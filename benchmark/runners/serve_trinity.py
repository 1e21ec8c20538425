"""Drive a served Trinity-Mini model (model_type "afmoe": window-attention
layers that rotate beside full-attention layers that carry no position, a
q/k norm over each head, a sigmoid gate on the attention output, a norm
after each branch as well as before it, leading dense layers, then
sigmoid-routed experts beside a shared one, the embedding scaled by
sqrt(hidden)): runners/serve.py's method, step for step, as runners/
serve_mimo.py does it for MiMo-V2-Flash (its Session's checks of the window
group and its slices of the window are imported), with what this block
needs of its own: the session deploys the replica whose reference is that
block's (replica_trinity.py), built from a configuration file whose
published keys are this family's (`layer_types`, `num_dense_layers`,
`num_experts`, `num_shared_experts`, `route_norm`, `route_scale`,
`score_func`, `mup_enabled`, a `head_dim` that is not hidden / heads, ...),
and the cell's closed loop dealt as context-latent's is (runners/
serve_kanana.py:closed_loop, imported: every seed offers every window the
same work).

The program is asked first whether it has the fields. One that has not (a
commit before the block was served) fails here, in seconds and before any
cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import checks_trinity, loadgen
from benchmark.runners import serve, serve_mimo
from benchmark.runners.serve_kanana import closed_loop
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "ffn_dim",        # one routed expert's width
    "intermediate_size": "dense_ffn_dim",      # the leading dense layers'
    "num_dense_layers": "n_dense_layers", "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "route_norm": "norm_topk_prob", "score_func": "router_score",
    "route_scale": "router_scale", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "sliding_window": "sliding_window",
    "layer_types": "layer_types", "tie_word_embeddings": "tie_embeddings"}
#: published keys whose one value the program builds
_MUST_BE = {"model_type": "afmoe", "hidden_act": "silu", "n_group": 1,
            "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
            "rope_scaling": None, "mup_enabled": True}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed; the head's width, which is NOT hidden / heads, for q, k
    and v; the window layers on the same key/value heads and rotary base as
    the published keys give (the family has one of each); the shared
    expert's width; the embedding's multiplier; then the file's own
    `program_fields`, which hold the four things the family's modelling
    code says and no key does. A key the program does not build is refused
    by name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["score_head_dim"] = out["value_head_dim"] = config["head_dim"]
    out["window_kv_heads"] = config["num_key_value_heads"]
    out["window_rope_theta"] = config["rope_theta"]
    out["shared_ffn_dim"] = config["num_shared_experts"] \
        * config["moe_intermediate_size"]
    out["embed_scale"] = float(config["hidden_size"]) ** 0.5
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads, head_dim 8) with tiny ones; this block's own are cut
    to match, in place: a leading dense window layer and one period of
    (window, full) in the three layers, a window of 16 tokens (two pages of
    8), few experts, a head twice hidden / heads."""
    config.update(
        num_hidden_layers=3, num_dense_layers=1, head_dim=16,
        layer_types=["sliding_attention"] * 2 + ["full_attention"],
        sliding_window=16, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=max(8, config["intermediate_size"] // 4))


class Session(serve_mimo.Session):
    """serve_mimo.Session (its warm-up's checks: no prefix hit taken with a
    window group present, and the group did free pages), deploying
    replica_trinity.ProbedTrinityServer with the fields above."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_trinity import ProbedTrinityServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedTrinityServer,
                                app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, the closed
    loop dealt by serve_kanana.closed_loop, and the scored tokens held to
    checks_trinity.py's limits (a CPU rehearsal computes in float32 and
    keeps checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_trinity.gap_summary(groups),
                      scored_requests=checks_trinity.request_shares(groups))
        return checks_trinity.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held, deal = serve.checks.served_tokens, loadgen.closed_loop
    loadgen.closed_loop = closed_loop
    if not ctx["rehearse"]:
        serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
        loadgen.closed_loop = deal
    data["notes"].update(scored)
    for key in ("window_pages_freed", "page_steps_full", "page_steps_window",
                "rows_inside_window", "decode_tokens", "moe_pairs",
                "chunk_rows", "chunk_rows_joined", "ragged_dispatches",
                "decode_dispatches"):
        a, b = data["stats_open"].get(key), data["stats_close"].get(key)
        data["notes"][key] = None if a is None or b is None else b - a
    # the window's edges on the wall clock the request log keeps
    wall_open = time.time() - (time.monotonic() - data["t_open"])
    data["notes"]["steady_state"] = serve_mimo.steady_state(
        data["request_log"], wall_open, data["window_s"],
        ctx["config"]["engine"]["max_batch"])
    return data
