"""Drive a served GigaChat3.5 model (model_type "gigachat3_5": gated-delta-rule
linear-attention layers whose float32 matrix state lives per batch slot,
every fourth layer latent attention with a low-rank query, YaRN frequencies
and an output gate, over ONE pool that holds the latent's page leaf beside
the delta layers' two state leaves; zero-centred gated norms before and
after every branch, clamped SwiGLUs, leading dense layers, then a held share
of 256 sigmoid-routed experts beside a shared one): runners/serve.py's
method, step for step, as runners/serve_granite.py does it for a block with
state per batch slot (its Session's check that no prefix hit was taken is
imported), with what this block needs of its own: the session deploys the
replica whose reference is that block's (replica_gigachat.py), built from a
configuration file whose published keys are this family's
(`full_attention_layers`, `linear_*`, `q_lora_rank`, `rope_scaling`,
`layernorm_gating_weight`, `swiglu_limit`, ...), and the cell's closed loop
dealt as context-latent's is (runners/serve_kanana.py:closed_loop, imported:
every seed offers every window the same work).

The program is asked first whether it has the fields. One that has not (a
commit before the block was served) fails here, in seconds and before any
cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import math
import time
from typing import Dict

from benchmark import checks_gigachat, kernel_cost_gigachat, loadgen
from benchmark.runners import serve, serve_granite
from benchmark.runners.serve_kanana import closed_loop
from benchmark.runners.serve_mimo import steady_state
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "ffn_dim",        # one routed expert's width
    "intermediate_size": "dense_ffn_dim",      # the leading dense layers'
    "first_k_dense_replace": "n_dense_layers",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "router_scale", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "linear_num_key_heads": "delta_key_heads",
    "linear_num_value_heads": "delta_value_heads",
    "linear_key_head_dim": "delta_key_dim",
    "linear_value_head_dim": "delta_value_dim",
    "linear_conv_kernel_dim": "delta_conv",
    "linear_attn_o_norm_eps": "delta_norm_eps",
    "tie_word_embeddings": "tie_embeddings"}
#: published keys whose one value the program builds
_MUST_BE = {"model_type": "gigachat3_5", "hidden_act": "silu",
            "attention_bias": False, "n_group": 1, "topk_group": 1,
            "rope_interleave": True,   # adjacent pairs: llama._rope_pairs
            "norm_type": "ZeroCenteredGatedNorm",
            "layernorm_type": "pre_post", "gated_attention": True,
            "use_shared_expert_sigmoid": False,
            "use_mla_scaling_factor": True,
            "linear_attention_type": "GigaChat35GatedDeltaNet",
            "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered"}
FULL, DELTA = "full_attention", "linear_attention"


def yarn_fields(config: Dict) -> Dict:
    """The program's ``rope_yarn`` and ``attn_scale`` from the published
    `rope_scaling` group: (factor, original positions, beta_fast, beta_slow,
    mscale, mscale_all_dim), and 1 / sqrt(qk_head_dim) times the square of
    m = 0.1 mscale_all_dim ln(factor) + 1 (use_mla_scaling_factor)."""
    rs = config["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"the program builds rope_scaling yarn only; this "
                         f"configuration has {rs['type']!r}")
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return {"rope_yarn": [rs["factor"],
                          rs["original_max_position_embeddings"],
                          rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                          rs["mscale_all_dim"]],
            "attn_scale": config["qk_head_dim"] ** -0.5 * m * m}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, the layers' kinds from `full_attention_layers`, the YaRN
    group, the chip's share of the experts, the shared expert's width, then
    the file's own `program_fields` (the readings under `assumed`, one line
    each). A key the program does not build is refused by name."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    latent = set(config["full_attention_layers"])
    out["layer_types"] = [FULL if i in latent else DELTA
                          for i in range(config["num_hidden_layers"])]
    out.update(yarn_fields(config))
    # the router's width is the PUBLISHED count; n_routed_experts counts
    # the experts held here
    first, n = config["experts_held"]
    if n != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts HELD here: "
                         "it is not experts_held's")
    out["n_experts"] = config.get("published", {}).get(
        "n_routed_experts", n)
    out["experts_held"] = [first, n]
    out["router_score"] = "sigmoid"
    out["shared_ffn_dim"] = config["n_shared_experts"] \
        * config["moe_intermediate_size"]
    # the readings of five keys (`assumed`): each ONE field, so a correction
    # is one line of the file's `program_fields`, which come last
    out["norm_gate"] = float(config["layernorm_gating_weight"])
    out["delta_gate_scale"] = float(config["linear_sigmoid_gate_scale"])
    out["ffn_clamp"] = float(config["swiglu_limit"])
    out["attn_gate"] = config["gated_attention"]
    out["post_norms"] = config["layernorm_type"] == "pre_post"
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads) with tiny ones; this block's own are cut to match,
    in place: a dense delta layer and one period of (delta, latent) in three
    layers, a latent two lanes wide whose row is not a whole number of them
    (256 + 16), a low-rank query, few experts of which half are held, two
    value heads to a key head, a chunk form of 8 tokens a block."""
    config.update(
        num_hidden_layers=3, first_k_dense_replace=1,
        full_attention_layers=[2], kv_lora_rank=256, q_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=16, qk_head_dim=32,
        v_head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        n_routed_experts=4, num_experts_per_tok=3, experts_held=[2, 4],
        published={"n_routed_experts": 8},
        moe_intermediate_size=max(8, config["intermediate_size"] // 4))
    # float32 compute: a norm after every branch at a width of 64 lets
    # bf16 flip a fifth of twenty greedy tokens, and checks.py's limits
    # are held on those
    config["program_fields"] = {**config.get("program_fields", {}),
                                "delta_chunk": 8, "dtype": "float32"}


class Session(serve_granite.Session):
    """serve_granite.Session (its warm-up's check: no prefix hit taken with
    state per batch slot present), deploying
    replica_gigachat.ProbedGigaChatServer with the fields above; and the
    check that the pool is the ONE the configuration states: a latent leaf
    of at most 640 values a token beside, a batch slot, the delta layers'
    matrix state in FLOAT32 and their conv's inputs in the compute dtype,
    to the byte (kernel_cost_gigachat.state_bytes_per_slot)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_gigachat import ProbedGigaChatServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedGigaChatServer,
                                app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        width = stats.get("kv_row_width")
        compute = self.config.get("program_fields", {}).get("dtype",
                                                            "bfloat16")
        want = kernel_cost_gigachat.state_bytes_per_slot(
            kernel_cost_gigachat.model_dims(self.config),
            4 if compute == "float32" else 2)
        if width is None or width > 640 \
                or stats.get("state_bytes_per_slot") != want:
            raise RuntimeError(
                f"the pool is not a latent leaf of at most 640 values a "
                f"token beside {want} bytes of state a batch slot, the "
                f"matrix state float32 (kv_row_width {width}, "
                f"state_bytes_per_slot "
                f"{stats.get('state_bytes_per_slot')})")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, the closed
    loop dealt by serve_kanana.closed_loop, and the scored requests held to
    checks_gigachat.py's limits (a CPU rehearsal computes in float32 and
    keeps checks.py's on the tokens; the recurrent state's limit holds
    there too)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(scored_gaps=checks_gigachat.gap_summary(groups),
                      scored_requests=checks_gigachat.request_shares(groups),
                      # the worst; one not compared or not finite first
                      scored_state_error=max(
                          (s.get("state_error") for g in groups.values()
                           for s in g), default=None,
                          key=lambda e: math.inf if e is None or e != e
                          else e))
        if ctx["rehearse"]:
            return held(groups) + checks_gigachat.state_faults(groups)
        return checks_gigachat.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held, deal = serve.checks.served_tokens, loadgen.closed_loop
    loadgen.closed_loop = closed_loop
    serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
        loadgen.closed_loop = deal
    data["notes"].update(scored)
    for key in ("kv_row_width", "kv_token_layer_bytes", "state_bytes",
                "state_bytes_per_slot"):
        data["notes"][key] = data["stats_close"].get(key)
    for key in ("decode_tokens", "chunk_rows", "chunk_rows_joined",
                "ragged_dispatches", "decode_dispatches", "state_resets",
                "moe_pairs", "moe_absent"):
        a, b = data["stats_open"].get(key), data["stats_close"].get(key)
        data["notes"][key] = None if a is None or b is None else b - a
    # the window's edges on the wall clock the request log keeps
    wall_open = time.time() - (time.monotonic() - data["t_open"])
    data["notes"]["steady_state"] = steady_state(
        data["request_log"], wall_open, data["window_s"],
        ctx["config"]["engine"]["max_batch"])
    return data
