"""Drive a served LFM2 model: runners/serve.py's method, step for step, as
runners/serve_moe.py does it for OLMoE, with what a block whose LAYERS
DIFFER needs of its own: the session deploys the replica whose reference is
that block's (replica_lfm2.py), built from a configuration file whose
published keys are this family's (`layer_types`, `num_dense_layers`,
`moe_intermediate_size`, `rope_parameters.rope_theta`, `norm_eps`, ...).

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import checks_lfm2
from benchmark.readers._stats import lookup
from benchmark.runners import serve
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "moe_intermediate_size": "ffn_dim",        # one expert's width
    "intermediate_size": "dense_ffn_dim",      # the leading dense layers'
    "num_dense_layers": "n_dense_layers", "layer_types": "layer_types",
    "conv_L_cache": "conv_kernel", "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "use_expert_bias": "router_bias",
    "routed_scaling_factor": "router_scale",
    "rope_parameters.rope_theta": "rope_theta", "norm_eps": "norm_eps"}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, then the file's own `program_fields` (what the family's
    code implies and the published file does not state: see `assumed`)."""
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    if config["conv_bias"]:
        raise ValueError("the program's conv operator has no bias")
    out = {dst: lookup(config, src) for src, dst in _HF_TO_FIELDS.items()}
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads) with tiny ones; this block's own keys are cut to
    match, in place: both kinds of operator and both kinds of feed-forward
    in however few layers are left."""
    n = config["num_hidden_layers"]
    config["layer_types"] = (["conv", "full_attention"] * n)[:n]
    config["num_dense_layers"] = min(config["num_dense_layers"], n - 1)
    config["moe_intermediate_size"] = max(8, config["intermediate_size"] // 4)


class Session(serve.Session):
    """serve.Session, deploying replica_lfm2.ProbedLFM2Server with the
    fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_lfm2 import ProbedLFM2Server
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedLFM2Server, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the check that the engine took no prefix
        hit: with conv state a hit that restores KV alone is a wrong
        answer, so the warm-up's repeated prompt must have been computed
        whole."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if stats["cached_tokens"] or stats["cow_copies"]:
            raise RuntimeError(
                f"the engine took a prefix hit with conv layers present: "
                f"{stats['cached_tokens']} cached tokens")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, and the
    scored tokens held to this block's limits (checks_lfm2.py says why
    checks.py's cannot hold for it; a CPU rehearsal computes in float32
    and keeps checks.py's)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    scored = {}

    def served_tokens(groups):
        scored.update(checks_lfm2.gap_summary(groups))
        return checks_lfm2.served_tokens(groups)

    base, serve.Session = serve.Session, Session
    held = serve.checks.served_tokens
    if not ctx["rehearse"]:
        serve.checks.served_tokens = served_tokens
    try:
        data = serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
    if scored:
        data["notes"]["scored_gaps"] = scored
    return data
