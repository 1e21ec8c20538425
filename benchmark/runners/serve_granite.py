"""Drive a served granite-4.0-h model: runners/serve.py's method, step for
step, as runners/serve_lfm2.py does it for LFM2, with what a block of
STATE-SPACE layers needs of its own: the session deploys the replica whose
reference is that block's (replica_granite.py), built from a configuration
file whose published keys are this family's (`mamba_n_heads`,
`mamba_d_head`, `mamba_d_state`, `mamba_d_conv`, `mamba_chunk_size`, the
four multipliers, `position_embedding_type`, ...).

The program is asked first whether it has those fields. One that has not
(a commit before the block was served) fails here, in seconds and before
any cluster is up, with an exit code of its own.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmark import checks_granite
from benchmark.runners import serve
from benchmark.runners.serve_moe import require_program_support

#: the published config.json's keys -> the program's LlamaConfig fields
_HF_TO_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_dim",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_d_conv": "ssm_conv",
    "mamba_chunk_size": "ssm_chunk",
    "embedding_multiplier": "embed_scale",
    "residual_multiplier": "residual_scale",
    "attention_multiplier": "attn_scale", "logits_scaling": "logits_divisor"}
#: published keys whose one value the program builds
_MUST_BE = {"mamba_n_groups": 1, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "num_local_experts": 0, "position_embedding_type": "nope",
            "hidden_act": "silu", "normalization_function": "rmsnorm"}
#: the published names of the operators -> the program's
_OPERATORS = {"mamba": "mamba", "attention": "full_attention"}


def model_fields(config: Dict) -> Dict:
    """The program's model_config for a configuration file: the published
    keys renamed, the operators' names translated, no positional embedding
    (`position_embedding_type` "nope"), then the file's own
    `program_fields`."""
    for key, want in _MUST_BE.items():
        if config[key] != want:
            raise ValueError(f"the program builds {key} = {want!r} only; "
                             f"this configuration has {config[key]!r}")
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration's differs")
    if config["mamba_expand"] * config["hidden_size"] \
            != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads "
                         "x mamba_d_head")
    if config["shared_intermediate_size"] != config["intermediate_size"]:
        raise ValueError("the shared MLP is the whole feed-forward: its "
                         "width must be intermediate_size")
    out = {dst: config[src] for src, dst in _HF_TO_FIELDS.items()}
    out["layer_types"] = [_OPERATORS[t] for t in config["layer_types"]]
    out["rope"] = False
    out.update(config.get("program_fields", {}))
    return out


def cut_for_rehearsal(config: Dict) -> None:
    """The selftest overrides the keys every configuration has (hidden
    size, depth, heads) with tiny ones; this block's own keys are cut to
    match, in place: both kinds of operator in however few layers are
    left, a state of 8 heads of hidden / 4 and 16 columns."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    config["layer_types"] = (["mamba", "attention"] * n)[:n]
    config["shared_intermediate_size"] = config["intermediate_size"]
    config.update(mamba_n_heads=8, mamba_d_state=16, mamba_chunk_size=8,
                  mamba_d_head=config["mamba_expand"] * d // 8)


class Session(serve.Session):
    """serve.Session, deploying replica_granite.ProbedGraniteServer with
    the fields above (cluster.deploy_llm's steps; only the class that
    build_llm_app binds is swapped)."""

    def __init__(self, config: Dict, seed: int):
        from ray_tpu import serve as rt_serve
        from ray_tpu.llm import build_llm_app

        from benchmark.replica_granite import ProbedGraniteServer
        self.config = config
        engine = {**config["engine"], "seed": int(seed) % (2 ** 31)}
        dep = config.get("deployment_settings", {})
        t0 = time.monotonic()
        app = build_llm_app(model_fields(config), engine, name=serve.MODEL,
                            max_ongoing_requests=dep.get(
                                "max_ongoing_requests", 16))
        app = rt_serve.Application(
            rt_serve.Deployment(ProbedGraniteServer, app.deployment._config),
            app.args, app.kwargs)
        self.handle = rt_serve.run(app, timeout_s=900)
        self.ready_s = time.monotonic() - t0
        self.port = rt_serve.start_http_proxy()

    def warm_and_score(self, mix: Dict, seed: int) -> Dict:
        """serve.Session's, and the check that the engine took no prefix
        hit: with state per batch slot a hit that restores KV alone is a
        wrong answer, so the warm-up's repeated prompt must have been
        computed whole."""
        out = super().warm_and_score(mix, seed)
        stats = self.probe()["stats"]
        if stats["cached_tokens"] or stats["cow_copies"]:
            raise RuntimeError(
                f"the engine took a prefix hit with state-space layers "
                f"present: {stats['cached_tokens']} cached tokens")
        return out


def run(ctx: Dict) -> Dict:
    """serve.run with this module's Session in Session's place, and the
    scored tokens held to checks.py's limits and this block's tighter
    tolerance beside them (checks_granite.py says what both read on the
    chip)."""
    if ctx["rehearse"]:
        cut_for_rehearsal(ctx["config"])
    require_program_support(model_fields(ctx["config"]))
    base, serve.Session = serve.Session, Session
    held = serve.checks.served_tokens
    serve.checks.served_tokens = checks_granite.served_tokens
    try:
        return serve.run(ctx)
    finally:
        serve.Session, serve.checks.served_tokens = base, held
